"""Client clock, request sent -> first token frame: the nearest-rank
90th percentile over the first tokens that arrived inside the window
(~100 samples: the tenth largest). A failed request is an infinite
sample."""

from benchmark import clientstats, traffic


def read(c):
    samples = clientstats.ttft_ms(c)
    if not samples:
        return None
    return clientstats.finite(traffic.percentile(samples, 90))
