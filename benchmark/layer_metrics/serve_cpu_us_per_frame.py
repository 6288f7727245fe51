"""CPU microseconds that the served process's Python threads other than
the engine's spend a token frame: the window's growth of on-CPU seconds
in ``engine_stats()["threads"]`` (``_private/profiler.py``
``thread_cpu``: the scheduler's own record a task, by thread group),
every group but ``llm-engine``, ``MainThread`` (the benchmark's driver,
asleep through the window) and ``native`` (tasks that are no Python
thread and take no interpreter: the XLA and TPU runtimes'), over the
growth of ``phase_hist["stream_hold"]["count"]``, one a frame that left
the replica. The proxy's loop, the pollers, the runtime's loop and the
actors' call slots: what serving costs beside the engine, and all of it
competes with the engine's thread for one interpreter.

``per_frame`` is shared with ``runtime_cpu_us_per_frame``. A program
whose ``engine_stats`` has no ``threads``, as every commit before PR 60,
gives nothing to read."""

from benchmark import harness

NOT_SERVING = ("llm-engine", "MainThread", "native")


def frames(stats) -> int:
    a, b = ((s.get("phase_hist", {}).get("stream_hold") or {"count": 0})
            ["count"] for s in stats)
    return b - a


def growth(stats, field: str, keep) -> float:
    """Window growth of ``field`` summed over the groups ``keep``
    accepts; None where either reading lacks the table."""
    tables = [(s.get("threads") or {}).get("by_group") for s in stats]
    if None in tables:
        return None
    a, b = tables
    total = 0.0
    for group, now in b.items():
        if not keep(group):
            continue
        was = a.get(group, {}).get(field, 0.0)
        if now.get(field) is None or was is None:
            return None
        total += now[field] - was
    return total


def per_frame(c, keep):
    stats = c.get("engine_stats")
    if not stats:
        return None
    cpu_s = growth(stats, "cpu_s", keep)
    n = frames(stats)
    return cpu_s / n * 1e6 if cpu_s is not None and n > 0 else None


def read(c):
    stats = c.get("engine_stats")
    if stats and all((s.get("threads") or {}).get("by_group") for s in stats):
        # The table's own check: its growth against the process's clock.
        total = growth(stats, "cpu_s", lambda g: True)
        clock = (stats[1]["threads"]["process_cpu_s"]
                 - stats[0]["threads"]["process_cpu_s"])
        harness.log(f"CPU by thread over the window: the groups grew "
                    f"{total:.3f} s, the process's CPU clock {clock:.3f} s")
    return per_frame(c, lambda g: g not in NOT_SERVING)
