"""Median host time of a prefill chunk's dispatch, from the chunk
span's start until the chunk program's call has returned: the fourth
field of every row of ``prefill_chunks`` in the window's ``llm.step``
ring entries (the third is the whole span, ``prefill_chunk_ms``)."""

from benchmark import timeline


def read(c):
    return timeline.median_or_none(
        [chunk[3] for e in timeline.entries(c, "prefill_chunks")
         for chunk in e["prefill_chunks"] if len(chunk) > 3])
