"""Median device span of one prefill chunk of a model with latent
attention (its forward pass in the up-projecting form over the cached
latent rows, with the pool write inside it), host clock from dispatch
to results ready: the third field of every row of ``prefill_chunks`` in
the window's ``llm.step`` ring entries. What ``prefill_chunk_ms`` reads,
under a name of its own because that metric moves ``ttft_p50_ms``,
which a cell of 16,384-token documents does not report: there the
chunks' spans decide how many decode steps a second are left, so this
one moves ``serve_tokens_per_s``."""

from benchmark import timeline


def read(c):
    return timeline.median_or_none(
        [chunk[2] for e in timeline.entries(c, "prefill_chunks")
         for chunk in e["prefill_chunks"]])
