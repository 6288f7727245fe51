"""Mean time of a request in the proxy between its arrival at the
handler and its dispatch to a replica, over the window's requests:
``engine_stats()["phase_hist"]["proxy_queue"]`` (the proxy shares the
replica's process in the serving cells, so its phases are in the
deployment's histogram). It is the part of a first token's wait that
the proxy's loop and its executor hand-off cost before the engine has
seen the request."""

from benchmark import timeline


def read(c):
    return timeline.hist_mean_ms(c, "proxy_queue")
