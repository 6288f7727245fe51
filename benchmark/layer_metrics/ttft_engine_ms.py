"""Mean time from ``add_request`` to the first token leaving the
replica's generator, over the first tokens of the window:
``engine_stats()["phase_hist"]["ttft"]``. The client's ``ttft_p50_ms``
less this is the proxy, the pull and the wire."""

from benchmark import timeline


def read(c):
    return timeline.hist_mean_ms(c, "ttft")
