"""Mean time a stream chunk sat in the replica between its generator
yielding it and the pull that carries it returning
(``Replica.stream_next``), over the chunks of the window:
``engine_stats()["phase_hist"]["stream_hold"]``."""

from benchmark import timeline


def read(c):
    return timeline.hist_mean_ms(c, "stream_hold")
