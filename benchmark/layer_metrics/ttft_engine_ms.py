"""Mean time from ``add_request`` to the engine's hand-over of the
first token to the replica (since PR 52 the ``ttft`` phase is recorded
there, on the engine's thread; before it, on the stream's feeder thread
once it had pulled the token, so values across PR 52 are not one
quantity), over the first tokens of the window:
``engine_stats()["phase_hist"]["ttft"]``. The client's ``ttft_p50_ms``
less this is the proxy, the replica's reply and the wire."""

from benchmark import timeline


def read(c):
    return timeline.hist_mean_ms(c, "ttft")
