"""Milliseconds a step that the engine's thread was runnable and waited
for a CORE: the window's growth of the ``llm-engine`` group's run-queue
wait in ``engine_stats()["threads"]`` (the scheduler's own record,
``/proc/self/task/<tid>/schedstat``) over the window's steps. It is the
part of ``engine_stall_ms`` in which the OS, not the interpreter, kept
the thread off a core; the rest of a stall is a wait for the
interpreter or a lock. Nothing to read where the kernel keeps no
``schedstat`` (the log says so), nor on a program without the table.

The benchmark's own host is such a kernel (PERF.md section 6, PR 60),
so ``BENCHMARK.json`` does not list this metric yet: a listed metric
has to be reported. It reads on a host that keeps the record."""

from benchmark import harness


def read(c):
    stats = c.get("engine_stats")
    steps = len(c.get("engine_steps") or [])
    if not stats or not steps:
        return None
    groups = [((s.get("threads") or {}).get("by_group") or {})
              .get("llm-engine") for s in stats]
    if None in groups:
        return None
    a, b = (g.get("wait_s") for g in groups)
    if a is None or b is None:
        harness.log("engine_runq_wait_ms: this host's kernel keeps no "
                    "schedstat: no run-queue wait to read")
        return None
    return (b - a) * 1e3 / steps
