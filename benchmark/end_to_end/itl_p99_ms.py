"""Gap between consecutive token frames of one stream at the client,
all streams pooled: the nearest-rank 99th percentile. The proxy hands
a stream's tokens on in pulls of up to 16, so one gap in sixteen is a
pull's and the rest are a step's or none: the 99th percentile lies well
inside the pulls' gaps, the 95th on the edge between the two kinds."""

from benchmark import clientstats, traffic
from benchmark.harness import log


def read(c):
    samples = clientstats.gaps_ms(c)
    if not samples:
        return None
    log(f"itl_p99_ms: {len(samples)} samples" if c["rehearse"] else
        f"itl_p99_ms: {len(samples)} samples; p50 "
        f"{traffic.percentile(samples, 50):.1f}, p90 "
        f"{traffic.percentile(samples, 90):.1f}, p95 "
        f"{traffic.percentile(samples, 95):.1f}, p99 "
        f"{traffic.percentile(samples, 99):.1f}, max {max(samples):.1f} ms")
    return clientstats.finite(traffic.percentile(samples, 99))
