"""Gap between consecutive token frames of one stream at the client,
all streams pooled: the nearest-rank 95th percentile (ISSUE 23's tail;
see ``end_to_end/itl_p99_ms.py`` for why the 99th carries the bound)."""

from benchmark import clientstats, traffic


def read(c):
    samples = clientstats.gaps_ms(c)
    if not samples:
        return None
    return clientstats.finite(traffic.percentile(samples, 95))
