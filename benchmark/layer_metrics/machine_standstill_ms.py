"""Milliseconds of the window in which the whole process stood still:
the sum of ``standstill_ms`` over the window's steps. The interpreter
probe puts a sample's lateness there when it ran again at least 50 ms
late and the PROCESS's CPU clock had advanced by under a tenth of the
sample's wall time: no thread of the process ran, so the OS or the
machine had it (a pause in which a thread did run, a collection's pass
for one, is ``held_long_ms`` of the same entry). 0 in a clean run; in a
run that reads slow it says whether the slow step was the machine's.

One reader for ``machine_standstill_ms.serve`` (the ``llm.step`` ring
entries) and ``machine_standstill_ms.train`` (the reports' rows, where
``train/session.py`` hands the ring entry's key on as
``train_standstill_ms``), which differ in the metric they move. A
program without the probe gives nothing to read."""

from benchmark import timeline


def read(c):
    spans = [r["train_standstill_ms"] for r in c.get("reports") or []
             if "train_standstill_ms" in r] \
        or [e["standstill_ms"] for e in timeline.entries(c, "standstill_ms")]
    return sum(spans) if spans else None
