"""Median device span of one prefill chunk (its forward pass with the
pool write dispatched inside it), host clock from dispatch to logits
ready: the third field of every row of ``prefill_chunks`` in the
window's ``llm.step`` ring entries."""

from benchmark import timeline


def read(c):
    return timeline.median_or_none(
        [chunk[2] for e in timeline.entries(c, "prefill_chunks")
         for chunk in e["prefill_chunks"]])
