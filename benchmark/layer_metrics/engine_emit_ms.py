"""Median host time an engine step spends handing tokens on: queue
pushes, finishes and block release, the ``llm.emit`` phase of the
window's ``llm.step`` ring entries."""

from benchmark import timeline


def read(c):
    return timeline.phases_ms(c, ("llm.emit",))
