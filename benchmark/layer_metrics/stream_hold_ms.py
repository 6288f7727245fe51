"""Mean time a stream chunk sat in the replica between the hand-over
that filled its stream (``serve/replica.py`` ``push``, once a step
since PR 52; a generator deployment's yield) and the reply that
carries it leaving (``Replica.stream_poll``: one long-poll a handle
for every ready chunk, no pull of sixteen since PR 35), over the
chunks of the window: ``engine_stats()["phase_hist"]["stream_hold"]``."""

from benchmark import timeline


def read(c):
    return timeline.hist_mean_ms(c, "stream_hold")
