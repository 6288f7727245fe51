"""Median host time an engine step spends fetching the logits and
sampling every lane: the ``llm.sample`` phase of the window's
``llm.step`` ring entries."""

from benchmark import timeline


def read(c):
    return timeline.phases_ms(c, ("llm.sample",))
