"""Mean time of a ``stream_poll`` reply's way out:
``engine_stats()["phase_hist"]["stream_out"]``, once a reply, from the
instant it left the replica (where ``stream_hold`` ends) to the end of
the proxy loop's callback that wrote its streams' shares to their
sockets: result serialisation, the runtime's loop, the poller's wake,
``call_soon_threadsafe``, the loop's wake, encode and ``send``. Every
token frame pays it after its hold. A program that records no
``stream_out``, as every commit before PR 60, gives nothing to read."""

from benchmark import timeline


def read(c):
    return timeline.hist_mean_ms(c, "stream_out")
