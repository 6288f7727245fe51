"""Client clock, request sent -> first token frame: the nearest-rank
median over the first tokens that arrived inside the window. A failed
request is an infinite sample."""

from benchmark import clientstats, traffic
from benchmark.harness import log


def read(c):
    samples = clientstats.ttft_ms(c)
    if not samples:
        return None
    log(f"ttft_p50_ms: {len(samples)} samples" if c["rehearse"] else
        f"ttft_p50_ms: {len(samples)} samples; p50 "
        f"{traffic.percentile(samples, 50):.1f}, p90 "
        f"{traffic.percentile(samples, 90):.1f}, max {max(samples):.1f} ms")
    return clientstats.finite(traffic.percentile(samples, 50))
